"""The Trinity-Mini configuration, its mix, driver, cost function and trace
patterns, held to each other and to the catalog the configuration was copied
from (where this machine has it)."""

import json
import os
import re
import types

import pytest

import harness

FILES = harness.Files()
CONFIG = FILES.json("configs", "trinity_mini.json")
TRAFFIC = FILES.json("traffic", "s8192_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "trinity_mini_s8192_1chip"
REF = FILES.module("reference", "trinity_mini.py")


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_program_args_say_what_the_configurations_keys_say():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "trinity" and a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 2048
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 5
    assert int(a["--lm-dense-layers"]) == CONFIG["num_dense_layers"] == 1
    assert int(a["--lm-dense-ffn-dim"]) == CONFIG["intermediate_size"] == 6144
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] == 32
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] == 4
    assert int(a["--lm-head-dim"]) == CONFIG["head_dim"] == 128
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 25024
    assert int(a["--lm-experts"]) == CONFIG["num_experts_published"] == 128
    assert int(a["--lm-experts-held"]) == CONFIG["experts_held"] \
        == CONFIG["num_experts"] == 16
    assert int(a["--lm-moe-top-k"]) == CONFIG["num_experts_per_tok"] == 8
    assert int(a["--lm-ffn-dim"]) == CONFIG["moe_intermediate_size"] == 1024
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16"
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == 8192 <= CONFIG["max_position_embeddings"]
    assert int(t["--batch-size"]) == 2


def test_reduced_and_the_held_values_agree():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "num_dense_layers": 2, "num_experts": 128,
                                   "vocab_size": 200192}
    for key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key], key
        assert key in CONFIG["cut"], key
    assert "8 chips share each layer" in CONFIG["deployment"]
    # the floors: a leading dense layer and a whole period of the layer
    # pattern after it, at least 8 routed experts, an eighth of the vocabulary
    n = CONFIG["num_hidden_layers"]
    kinds = CONFIG["layer_types"][:n]
    assert kinds == ["sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention",
                     "sliding_attention"]
    period = CONFIG["global_attn_every_n_layers"]
    assert n - CONFIG["num_dense_layers"] == period == 4
    assert sorted(kinds[1:]) == sorted(CONFIG["layer_types"][:period])
    assert len(CONFIG["layer_types"]) == 32     # carried whole
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["trinity"]
    assert row.window == CONFIG["sliding_window"] == 2048
    assert row.rope_theta == CONFIG["rope_theta"] == 10000
    assert row.norm_eps == CONFIG["rms_norm_eps"] == 1e-5
    assert row.gate_norm is CONFIG["route_norm"] is True
    assert row.route_scale == CONFIG["route_scale"] == 2.826
    assert row.router_score == CONFIG["score_func"] == "sigmoid"
    assert row.router_bias_rate == CONFIG["load_balance_coeff"] == 0.001
    assert row.shared_experts == CONFIG["num_shared_experts"] == 1
    assert row.embed_scale is CONFIG["mup_enabled"] is True
    assert row.expert_act == CONFIG["hidden_act"] == "silu"
    assert row.aux_coef == 0.0 == row.z_loss_coef
    assert row.rms_norm and row.dropless and row.head_qk_norm
    assert row.attn_gate and row.post_norm
    assert not row.qk_norm and not row.early_router
    types_ = CONFIG["layer_types"]
    assert [t == "sliding_attention" for t in types_] \
        == [row.layer_window(i) is not None for i in range(32)] \
        == [row.layer_rope(i) for i in range(32)]


def test_every_published_key_is_carried_reduced_or_assumed():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Trinity-Mini"]
    if not row:
        pytest.skip("this machine's catalog has no Trinity-Mini row")
    assert CONFIG["source"] == row[0]["source_url"]
    for key, value in row[0]["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] < value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    # what the catalog's config has no key for is assumed, each with its source
    for name in ("output_gate", "qk_norm_per_head", "four_norms",
                 "nope_on_global_layers", "bias_in_selection_only",
                 "bias_update", "gates_on_expert_outputs", "swiglu",
                 "optimizer", "no_auxiliary_loss", "initialisers", "data"):
        assert len(CONFIG["assumed"][name]) > 40, name


def test_reference_counts_the_published_model_and_the_cut():
    published = dict(CONFIG, **CONFIG["published"], experts_held=128)
    assert REF.param_count(published) == CONFIG["parameters_published"] \
        == 26_123_970_560
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512          # q, gate, o; k, v
    norms = 4 * 2048 + 2 * 128
    expert = 3 * 2048 * 1024
    routing = 2048 * 128 + expert + 16 * expert           # router, shared, held
    assert REF.param_count(CONFIG) == CONFIG["parameters_as_run"] \
        == 5 * (attention + norms) + 3 * 2048 * 6144 + 4 * routing \
        + 2 * 25024 * 2048 + 2048 == 705_473_792
    macs = 5 * attention + 2 * 4096 * (4 * 1792.125 + 4096.5) \
        + 3 * 2048 * 6144 + 4 * (expert + 2048 * 128 + 8 * 16 / 128 * expert) \
        + 2048 * 25024
    assert REF.keys_per_query(8192, 2048) == 1792.125
    assert REF.train_flops_per_sample(CONFIG, seq_len=8192, batch=2) \
        == pytest.approx(6 * macs, rel=1e-12)
    assert 2.21e9 < 6 * macs < 2.22e9


def test_closed_form_flops_against_a_traced_count_at_a_tiny_size():
    """The walk of the program's jaxpr finds the closed form's projections,
    dense layer, shared expert, router and head; attention dense S x S
    (``full_attention`` multiplies what it then masks) and the routed experts
    on every sorted row the held part is sized for. With those two parts
    exchanged the forward agrees exactly."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models import transformer as tr_mod
    from ps_pytorch_tpu.models.moe import MoETransformerLM
    from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

    s = 32
    tiny = dict(CONFIG, hidden_size=24, head_dim=8, num_attention_heads=4,
                num_key_value_heads=2, sliding_window=8, intermediate_size=40,
                moe_intermediate_size=16, num_experts_per_tok=3,
                num_experts=4, num_experts_published=8, experts_held=4,
                vocab_size=97)
    row = tr_mod.ARCHS["trinity"]
    tr_mod.ARCHS["trinity"] = row._replace(window=8)
    try:
        model = MoETransformerLM(
            vocab_size=97, n_layers=5, n_heads=4, kv_heads=2, head_dim=8,
            d_model=24, max_seq_len=s, arch="trinity", ffn_dim=16,
            n_experts=8, top_k=3, experts_held=4, dense_layers=1,
            dense_ffn_dim=40)
        tokens = jnp.zeros((2, s), jnp.int32)
        variables = model.init(jax.random.key(0), tokens)
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: model.apply(v, tokens)[0])(variables).jaxpr)
    finally:
        tr_mod.ARCHS["trinity"] = row
    parts = REF.macs_per_token(tiny, s)
    assert parts["attention"] == 2 * 32 * (
        REF.keys_per_query(s) + 4 * REF.keys_per_query(s, 8))
    assert parts["experts"] == 4 * 3 * (4 / 8) * 3 * 24 * 16
    assert parts["shared"] == 4 * 3 * 24 * 16
    assert parts["dense"] == 3 * 24 * 40
    # the held part's rows: 1.5 x T*k*held/E in whole tiles of 512, capped at T*k
    rows = min(2 * s * 3, 512)
    walked_parts = dict(parts, attention=5 * 2 * 32 * s,
                        experts=4 * rows / (2 * s) * 3 * 24 * 16)
    assert walked == 2 * sum(walked_parts.values()) * tokens.size


def _trainer():
    cfg = types.SimpleNamespace(
        batch_size=2, lm_seq_len=8192, lm_heads=32, lm_kv_heads=4,
        lm_head_dim=128, lm_d_model=2048, lm_layers=5, lm_dense_layers=1,
        lm_dense_ffn_dim=6144, lm_experts=128,
        lm_experts_held=16, lm_moe_top_k=8, lm_ffn_dim=1024,
        lm_arch="trinity", log_every=1)
    return types.SimpleNamespace(cfg=cfg)


def _shape(**kw):
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    return dict(driver.shape(_trainer()), activation_dtypes=["bfloat16"], **kw)


def test_driver_shape_says_heads_windows_layers_and_the_share():
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.THROUGHPUT == "tokens_per_s"
    assert driver.samples_per_step(_trainer()) == 16384
    shape = _shape()
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) \
        == (32, 4, 128)
    assert shape["windows"] == [2048, 2048, 2048, 0, 2048]
    assert (shape["experts"], shape["experts_held"], shape["top_k"]) \
        == (128, 16, 8)
    assert (shape["layers"], shape["moe_layers"], shape["dense_layers"],
            shape["dense_ffn_dim"], shape["shared_width"]) \
        == (5, 4, 1, 6144, 1024)
    short = _trainer()
    short.cfg.lm_seq_len = 2048          # the window never closes
    assert driver.shape(short)["windows"] == [0] * 5


def test_routed_grouped_matmul_cost_against_a_hand_count():
    cost = FILES.module("kernel_costs", "moe_grouped_matmul_routed.py")
    flops, nbytes = cost.required_per_step(_shape())
    rows = 16384 * 8 // 8
    assert flops == 4 * 9 * 2 * rows * 2048 * 1024
    assert nbytes == 4 * 9 * (16 * 2048 * 1024 * 4 + rows * (2048 + 1024) * 2)
    # the older count multiplies by every layer: a quarter too high here
    held = FILES.module("kernel_costs", "moe_grouped_matmul_held.py")
    assert held.required_per_step(_shape())[0] * 4 == flops * 5
    # and where every layer routes the two agree
    every = _shape()
    every.pop("moe_layers")
    assert cost.required_per_step(every) == held.required_per_step(every)


def test_flash_costs_hold_for_eight_heads_a_group_and_this_window():
    causal = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    window = FILES.module("kernel_costs", "flash_attention_gqa_window.py")
    band = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert causal.pairs(8192, 2048) == band
    flops_c, bytes_c = causal.required_per_step(_shape())
    flops_w, bytes_w = window.required_per_step(_shape())
    assert flops_c == 6 * 2 * 32 * (8192 * 8193 // 2) * 128 * 2
    assert flops_w == 4 * 6 * 2 * 32 * band * 128 * 2
    assert bytes_w == 4 * bytes_c == 4 * (
        6 * 2 * (32 + 4) * 8192 * 128 * 2 + 3 * 2 * 32 * 8192 * 4)


# HLO texts as the v5e's compiler names them at the cell's shape (compiled for
# a described v5e, PR 31).
TRACE_TEXTS = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "trinity_trace_texts.json")))
PATTERNS = {
    "flash_win_ms_per_step": {"window"}, "flash_win_roofline": {"window"},
    "flash_gqa_roofline": {"global"},
    "flash_fwd_ms_per_step": {"global"}, "flash_bwd_ms_per_step": {"global"},
    "moe_gmm_routed_roofline": {"gmm"}, "moe_gmm_ms_per_step": {"gmm"},
    "moe_dispatch_s8192_ms_per_step": {"dispatch"},
    "moe_shared_ms_per_step": {"shared"},
}


@pytest.mark.parametrize("metric", sorted(PATTERNS))
def test_trace_patterns_find_their_ops_and_no_others(metric):
    rx = re.compile(FILES.json("layer_metrics", metric + ".json")
                    ["params"]["pattern"])
    for kind, texts in TRACE_TEXTS.items():
        assert texts, kind
        for text in texts:
            hit = bool(rx.search(text))
            if metric == "flash_fwd_ms_per_step":
                assert hit == text.startswith("%flash_fwd"), text
            elif metric == "flash_bwd_ms_per_step":
                assert hit == text.startswith("%flash_bwd_dkv"), text
            else:
                assert hit == (kind in PATTERNS[metric]), (metric, text)


JOINED = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
          "flash_win_ms_per_step", "flash_win_roofline", "flash_gqa_roofline",
          "moe_gmm_ms_per_step", "expert_load_max_over_mean", "moe_dropped",
          "moe_held_share")
NEW = ("moe_gmm_routed_roofline", "moe_dispatch_s8192_ms_per_step",
       "moe_shared_ms_per_step", "moe_bias_abs_max",
       "moe_load_all_max_over_mean")
KEPT_OUT = ("moe_gmm_held_roofline", "moe_dispatch_held_ms_per_step",
            "flash_ms_per_step", "flash_roofline", "moe_gmm_roofline",
            "moe_dispatch_ms_per_step", "images_per_s", "conv_share",
            "allreduce_ms_per_step", "allreduce_exposed_ms")


def test_the_cells_name_is_in_exactly_the_lists_named():
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("trinity_mini", "s8192_1chip", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert listed == {"tokens_per_s", *JOINED, *NEW}
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s"} <= reports
    assert not set(KEPT_OUT) & reports
    # a later cell may join any of these lists (``listed`` above says this
    # cell is in them): no list is held to this cell alone
    for name in NEW:
        spec = FILES.json("layer_metrics", name + ".json")
        FILES.module("readers", spec["reader"] + ".py")
        if "cost" in spec["params"]:
            FILES.module("kernel_costs", spec["params"]["cost"] + ".py")


CONTROLS = harness.load_module(os.path.join(harness.HERE, "controls",
                                            "trinity_mini.py"))
# one control of each way of planting (arch, model and variables, the
# reference's variables, its route) and both precision controls;
# tests/test_trinity.py runs every one of them without the harness
PLANTED = ("gate_missing", "first_layer_routed_not_dense", "kv_head_h_mod_kv",
           "bias_weighs_too", "parameters_in_float8",
           "block_parameters_in_float8")


@pytest.fixture(scope="module")
def tiny_trainer():
    """What ``harness._reference_check`` and the driver read of a trainer, at
    a tiny float32 size: the model, its state, vocabulary and length."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models import transformer as tr_mod
    from ps_pytorch_tpu.models.moe import MOE_STATE, MoETransformerLM

    config = dict(CONFIG, hidden_size=24, head_dim=8, num_attention_heads=4,
                  num_key_value_heads=2, sliding_window=8,
                  intermediate_size=40, moe_intermediate_size=16,
                  num_experts_per_tok=3, num_experts=4,
                  num_experts_published=8, experts_held=4, vocab_size=97,
                  # float32 on both sides: reduction order, 4e-6 measured
                  reference_check={"samples": 1, "max_abs_logit_err": 1e-4})
    row = tr_mod.ARCHS["trinity"]
    tr_mod.ARCHS["trinity"] = row._replace(window=8)
    model = MoETransformerLM(
        vocab_size=97, n_layers=5, n_heads=4, kv_heads=2, head_dim=8,
        d_model=24, max_seq_len=32, arch="trinity", ffn_dim=16, n_experts=8,
        top_k=3, experts_held=4, dense_layers=1, dense_ffn_dim=40)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 32), jnp.int32))
    yield types.SimpleNamespace(
        model=model, cfg=types.SimpleNamespace(lm_vocab=97, lm_seq_len=32),
        state=types.SimpleNamespace(params=variables["params"],
                                    batch_stats=variables[MOE_STATE])), config
    tr_mod.ARCHS["trinity"] = row


@pytest.mark.parametrize("name", ("as_run",) + PLANTED)
def test_the_harness_comparison_fails_each_planted_control(tiny_trainer, name):
    """Through ``harness._reference_check`` itself, as the chip reading of
    ``controls/trinity_mini.py`` goes: the program as it runs is ``ok``, a
    planted control is not."""
    trainer, config = tiny_trainer
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    with CONTROLS.planted(CONTROLS.CONTROLS.get(name, {}), driver, REF,
                          config) as (d, r):
        check = harness._reference_check(d, r, trainer, config, seed=31)
    assert check["tolerance"] == 1e-4 and check["logit_scale"] > 2
    assert check["ok"] == (name == "as_run"), check


# What the comparison cannot see at the cell's size (PR 31, v5e): the maximum
# over tokens sits on the tokens whose 8th and 9th expert change places, and
# these four move every token by less than such a token moves.
UNSEEN = {"bias_weighs_too", "gates_not_scaled", "qk_norm_over_d_not_a_head",
          "block_parameters_in_float8"}


def test_the_chip_readings_hold_the_limit_between_them():
    """``controls/trinity_mini_s8192_1chip.json``: what the command line of
    ``controls/trinity_mini.py`` and the cell's own runs read on the v5e at
    the arch's embedding scale, through ``harness._reference_check``. The
    limit lies over every reading of the program as it runs and under every
    parameter in float8; of the other controls all but ``UNSEEN`` fail it."""
    from ps_pytorch_tpu.models.transformer import ARCHS
    readings = [r for r in harness.load_json(os.path.join(
        harness.HERE, "controls", CELL + ".json"))
        if r["embed_std"] == ARCHS["trinity"].embed_std]
    limit = CONFIG["reference_check"]["max_abs_logit_err"]
    as_run = [r["max_abs_err"] for r in readings if r["control"] == "as_run"]
    assert len(as_run) >= 10 and max(as_run) < limit / 1.5
    for name in CONTROLS.CONTROLS:
        got = [r["max_abs_err"] for r in readings if r["control"] == name]
        assert got, name
        assert (min(got) > limit) == (name not in UNSEEN), (name, got)
    float8 = [r["max_abs_err"] for r in readings
              if r["control"] == "parameters_in_float8"]
    assert min(float8) > 1.5 * limit


def test_the_new_readers_return_nothing_on_a_program_without_the_counters():
    """The parent logs neither counter and has no shared expert: the readers
    give no number and raise nothing."""
    run = types.SimpleNamespace(
        window_records=[{"step": 1, "loss": 1.0, "moe_dropped": 0.0}],
        steady=lambda: (types.SimpleNamespace(
            matching_seconds=lambda pattern, window: 0.0), (0.0, 1.0), 3))
    for name in NEW:
        spec = FILES.json("layer_metrics", name + ".json")
        reader = FILES.module("readers", spec["reader"] + ".py")
        assert reader.read(run, **spec["params"]) is None, name
