"""LR schedules (optim/schedules.py) and their TrainConfig/optimizer wiring
(VERDICT r1 item 7; reference surface: a constant lr grid-swept by
``tune.sh:1-36``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.optim.schedules import (
    build_schedule, cosine, step_decay, with_warmup,
)


def _at(sched, step):
    v = sched(jnp.asarray(step)) if callable(sched) else sched
    return float(v)


def test_step_decay_staircase():
    s = step_decay(0.1, decay_steps=10, gamma=0.5)
    assert _at(s, 0) == pytest.approx(0.1)
    assert _at(s, 9) == pytest.approx(0.1)
    assert _at(s, 10) == pytest.approx(0.05)
    assert _at(s, 25) == pytest.approx(0.025)


def test_cosine_endpoints_and_floor():
    s = cosine(0.2, total_steps=100, floor_factor=0.1)
    assert _at(s, 0) == pytest.approx(0.2)
    assert _at(s, 50) == pytest.approx((0.2 + 0.02) / 2)
    assert _at(s, 100) == pytest.approx(0.02)
    assert _at(s, 500) == pytest.approx(0.02)  # flat after horizon


def test_warmup_prefix_then_base():
    s = with_warmup(0.1, warmup_steps=5)
    # Linear ramp: (step+1)/5 * 0.1.
    assert _at(s, 0) == pytest.approx(0.02)
    assert _at(s, 4) == pytest.approx(0.1)
    assert _at(s, 17) == pytest.approx(0.1)
    # Warmup shifts a decaying base so decay starts AFTER the ramp.
    s2 = with_warmup(step_decay(0.1, 10, 0.5), warmup_steps=5)
    assert _at(s2, 14) == pytest.approx(0.1)     # base step 9 < 10
    assert _at(s2, 15) == pytest.approx(0.05)    # base step 10


def test_build_schedule_from_config():
    cfg = TrainConfig(lr=0.1, lr_schedule="constant")
    assert build_schedule(cfg) == 0.1
    cfg = TrainConfig(lr=0.1, lr_schedule="cosine", max_steps=40,
                      lr_decay_factor=0.0)
    s = build_schedule(cfg)
    assert _at(s, 40) == pytest.approx(0.0, abs=1e-9)
    with pytest.raises(ValueError):
        TrainConfig(lr_schedule="linear")


def test_scheduled_sgd_updates_shrink():
    """With a decaying schedule, later update magnitudes must shrink under
    constant gradients — through the real build_optimizer wiring."""
    cfg = TrainConfig(lr=0.5, lr_schedule="step", lr_decay_steps=2,
                      lr_decay_factor=0.1, momentum=0.0)
    tx = build_optimizer(cfg)
    params = {"w": jnp.ones((8,), jnp.float32)}
    state = tx.init(params)
    grads = {"w": jnp.ones((8,), jnp.float32)}
    deltas = []
    from ps_pytorch_tpu.parallel.dp import apply_optimizer
    for _ in range(4):
        new_params, state = apply_optimizer(tx, params, state, grads)
        deltas.append(float(jnp.abs(new_params["w"] - params["w"]).max()))
        params = new_params
    assert deltas[0] == pytest.approx(0.5)
    assert deltas[1] == pytest.approx(0.5)
    assert deltas[2] == pytest.approx(0.05)   # decayed at step 2
    assert deltas[3] == pytest.approx(0.05)


def test_trainer_accepts_schedule_end_to_end(tmp_path):
    """CLI surface: a cosine+warmup LeNet run through the Trainer must work
    and keep the STEP schema intact."""
    from ps_pytorch_tpu.runtime import Trainer

    cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet",
                      batch_size=64, lr=0.1, lr_schedule="cosine",
                      lr_warmup_steps=2, max_steps=6, eval_freq=0,
                      compute_dtype="float32",
                      train_dir=str(tmp_path / "ckpt"), resume=False,
                      log_every=100)
    t = Trainer(cfg)
    state = t.train()
    assert int(state.step[()] if hasattr(state.step, "__getitem__")
               else state.step) == 6


@pytest.mark.parametrize("s,rows,norm_rows", [(16384, 2048, 512),
                                              (1000, 1008, 512),
                                              (40, 48, 48)])
def test_the_mamba2_mixers_kernel_record_names_every_field(s, rows,
                                                           norm_rows):
    """The other kind of schedule: the static record of a kernel's tiles that
    ``LMTrainer`` prints on its ``KERNELS`` line. ``ssm_mix_schedule``'s
    (``ops/ssm_mix.py``, PR 45) says every field as ``name=value``, a grid
    as ``axbxc``, and its rows a step follow the sequence: 2048 (the norm's
    512, at four lane tiles a group), or a short sequence whole, in blocks of
    the 16 halo rows."""
    from ps_pytorch_tpu.ops.ssm_mix import ssm_mix_schedule
    sc = ssm_mix_schedule(2, s, 4096, 1024, 8, 4)
    assert (sc.rows, sc.norm_rows) == (rows, norm_rows)
    assert sc.rows % sc.halo == 0 and sc.rows % sc.chunk == 0 \
        and sc.norm_rows % sc.norm_chunk == 0
    said = dict(item.split("=") for item in sc.describe().split())
    assert list(said) == list(sc._fields)
    for name, value in sc._asdict().items():
        assert said[name] == ("x".join(map(str, value))
                              if isinstance(value, tuple) else str(value))


# (assignments T*k a layer, router outputs, experts held, d, f) of the five
# sparse cells -> (rows the layer sizes, fwd tiles, row tiles, of them owned
# at balance, visits)
GMM_CELLS = {
    "smallthinker_s16384_1chip": ((98304, 64, 16, 2560, 768),
                                  (36864, (256, 1280, 768), 144, 96, 160)),
    "trinity_mini_s8192_1chip": ((131072, 128, 16, 2048, 1024),
                                 (24576, (256, 2048, 1024), 96, 64, 112)),
    "nemotron3nano_s16384_1chip": ((98304, 128, 16, 2688, 1856),
                                   (18432, (256, 896, 1856), 72, 48, 88)),
    "qwen3next_s16384_1chip": ((163840, 512, 64, 2048, 512),
                               (30720, (256, 2048, 512), 120, 80, 184)),
    "olmoe_s4096_1chip": ((32768, 64, 64, 2048, 1024),
                          (32768, (256, 2048, 1024), 128, 128, 192)),
}


@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_the_grouped_matmuls_kernel_record_at_the_cells_shapes(cell):
    """``gmm_schedule`` (``ops/grouped_matmul.py``, PR 48), the ``KERNELS``
    line's record of an expert layer's grouped matmuls, as ``LMTrainer``
    calls it, at bfloat16 rows on float32 weights: a layer that holds a share
    sizes a third of its row tiles past what its groups own at balance
    (``models/moe.py:held_rows``, ``HELD_ROWS_SLACK``: those tiles are visited
    and not multiplied), OLMoE's none; every field is said as ``name=value``,
    a tile as ``tm/tk/tn``."""
    from ps_pytorch_tpu.models.moe import held_rows
    from ps_pytorch_tpu.ops.grouped_matmul import gmm_schedule
    (assignments, experts, held, d, f), (rows, fwd, tiles, owned, visits) = \
        GMM_CELLS[cell]
    assert held_rows(assignments, held, experts) == rows
    sc = gmm_schedule(rows, assignments * held // experts, d, f, held)
    assert (sc.fwd, sc.row_tiles, sc.row_tiles_at_balance, sc.visits) == \
        (fwd, tiles, owned, visits)
    assert sc.dlhs == (fwd[0], fwd[2], fwd[1])  # the same weights, read transposed
    assert sc.drhs[0] == fwd[0] and sc.weight_bytes == held * d * f * 4
    assert 3 * (tiles - owned) == (tiles if held < experts else 0)
    said = dict(item.split("=") for item in sc.describe().split())
    assert list(said) == list(sc._fields)
    for name, value in sc._asdict().items():
        assert said[name] == ("/".join(map(str, value))
                              if isinstance(value, tuple) else str(value))


# (tokens, top-k, router outputs, experts held, d) of the five cells that hold
# a share of their experts -> ``rows_schedule``'s record, letter for letter
ROWS_CELLS = {
    "granite4h_small_tp8_1chip": (
        (8192, 10, 72, 9, 4096),
        "tokens_tile=256 seg=16 segs=16 cols=512 tiles=32 rows=15360 "
        "rows_at_balance=10240 fwd_bytes=285212672 bwd_bytes=150994944"),
    "smallthinker_s16384_1chip": (
        (16384, 6, 64, 16, 2560),
        "tokens_tile=256 seg=16 segs=16 cols=512 tiles=64 rows=36864 "
        "rows_at_balance=24576 fwd_bytes=377487360 bwd_bytes=209715200"),
    "qwen3next_s16384_1chip": (
        (16384, 10, 512, 64, 2048),
        "tokens_tile=256 seg=16 segs=16 cols=512 tiles=64 rows=30720 "
        "rows_at_balance=20480 fwd_bytes=285212672 bwd_bytes=150994944"),
    "nemotron3nano_s16384_1chip": (
        (16384, 6, 128, 16, 2688),
        "tokens_tile=256 seg=16 segs=16 cols=384 tiles=64 rows=18432 "
        "rows_at_balance=12288 fwd_bytes=330301440 bwd_bytes=154140672"),
    "trinity_mini_s8192_1chip": (
        (16384, 8, 128, 16, 2048),
        "tokens_tile=256 seg=16 segs=16 cols=512 tiles=64 rows=24576 "
        "rows_at_balance=16384 fwd_bytes=268435456 bwd_bytes=134217728"),
}


@pytest.mark.parametrize("cell", sorted(ROWS_CELLS))
def test_the_held_rows_kernel_record_at_the_cells_shapes(cell):
    """``rows_schedule`` (``ops/moe_rows.py``, PR 52), the ``KERNELS`` line's
    ``moe_rows[...]`` record of the kernel that sums a held share's rows into
    their tokens, as ``LMTrainer`` calls it at bfloat16 rows: 256 tokens an
    output block, 16-row segments sixteen to a step's buffer, d in blocks of
    512 lanes (2688 = 7 x 384), the grid the token tiles, and the bytes the forward's call (the combine, which also adds the other
    part's float32 sum) and the backward's (the take's transpose) move at
    balance. A third of the rows sized are past what the groups own at
    balance: no DMA is started for them."""
    from ps_pytorch_tpu.models.moe import held_rows
    from ps_pytorch_tpu.ops.moe_rows import rows_schedule
    (tokens, k, experts, held, d), said = ROWS_CELLS[cell]
    rows = held_rows(tokens * k, held, experts)
    sc = rows_schedule(rows, tokens * k * held // experts, tokens, k, d,
                       jnp.bfloat16, held)
    assert sc.describe() == said
    assert 3 * (sc.rows - sc.rows_at_balance) == sc.rows
    assert sc.tiles * sc.tokens_tile == tokens and d % sc.cols == 0
    assert list(dict(item.split("=") for item in said.split())) == \
        list(sc._fields)
